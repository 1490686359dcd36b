"""The shipped rule families (docs/static_analysis.md is the catalog).

Every rule encodes an invariant a past PR paid for:

- ``lock.record-path`` / ``lock.ordering`` — the flight-recorder
  discipline (PRs 10/12) and lock-order safety across a class;
- ``retrace.*`` — the PR 6 retrace-storm class of bugs (unpinned
  ``out_shardings`` on mesh jits, unhashable statics, per-iteration
  re-jitting, non-canonical shape-cache keys);
- ``donation.read-after-dispatch`` — the PR 9 donated-buffer doctrine
  (a donated operand is DEAD after the call; XLA may have reused its
  buffer);
- ``shared.rmw`` — the thread-shared-state census: non-GIL-atomic
  read-modify-write on declared handler+driver classes must hold the
  class lock;
- ``deploy.swap-seam`` — the zero-downtime deploy doctrine (ISSUE 16):
  live weights are only rebound inside the drain seam
  (``__init__``/``swap_params``), never reached into from outside;
- ``metric.naming`` / ``metric.help`` — PR 5's Prometheus grammar
  (promoted from ``tests/test_observe.py::TestMetricNamingLint``) plus
  HELP-string presence per family.

All rules are intraprocedural by design: they check what a function's
own statements do, never what its callees do. That keeps every finding
explainable from the flagged line alone (and keeps the analyzer fast
enough to gate CI).
"""

import ast
import re

from veles_tpu.analyze.engine import Finding, Rule
from veles_tpu.analyze.registry import LOCK_ATTR_PATTERN
# the exposition regexes come from the runtime registry (the lockstep
# the deleted TestMetricNamingLint walk enforced): the gate must check
# exactly the grammar observe/metrics.py validates at booking time —
# metrics.py is stdlib-only, so the no-third-party constraint holds
from veles_tpu.observe.metrics import LABEL_NAME_RE, METRIC_NAME_RE

LOCK_ATTR_RE = re.compile(LOCK_ATTR_PATTERN, re.IGNORECASE)

#: calls forbidden on the record path: blocking, I/O, device sync
_RECORD_PATH_BANNED_NAMES = {"open", "print", "input"}
_RECORD_PATH_BANNED_ATTRS = {
    ("time", "sleep"): "blocks the record path",
    ("os", "replace"): "filesystem I/O",
    ("os", "rename"): "filesystem I/O",
    ("os", "remove"): "filesystem I/O",
    ("os", "unlink"): "filesystem I/O",
    ("os", "makedirs"): "filesystem I/O",
    ("os", "fsync"): "filesystem I/O",
    ("jax", "device_get"): "forces a device sync",
    ("jax", "block_until_ready"): "forces a device sync",
    ("jax", "effects_barrier"): "forces a device sync",
}
_DEVICE_SYNC_METHODS = {"block_until_ready"}
#: logging methods — handlers flush to streams/files, i.e. I/O
_LOGGING_METHODS = {"debug", "info", "warning", "error", "exception",
                    "critical"}


def _qualify(tree):
    """Map every function/class node to its dotted qualname (one level
    of class nesting is enough for this codebase)."""
    names = {}

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                qual = prefix + child.name if prefix else child.name
                names[child] = qual
                visit(child, qual + ".")
            else:
                visit(child, prefix)

    visit(tree, "")
    return names


def _dotted(node):
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_lockish(expr):
    """True for expressions that read like lock acquisition targets:
    ``self._lock``, ``some_mutex``, ``threading.Lock()`` results."""
    if isinstance(expr, ast.Call):
        dotted = _dotted(expr.func)
        if dotted and dotted.split(".")[-1] in (
                "Lock", "RLock", "Condition", "Semaphore",
                "BoundedSemaphore"):
            return True
        return False
    if isinstance(expr, ast.Attribute):
        return bool(LOCK_ATTR_RE.search(expr.attr))
    if isinstance(expr, ast.Name):
        return bool(LOCK_ATTR_RE.search(expr.id))
    return False


def _is_jit_call(node):
    """True for ``jax.jit(...)`` / bare ``jit(...)`` calls."""
    if not isinstance(node, ast.Call):
        return False
    dotted = _dotted(node.func)
    return dotted in ("jax.jit", "jit")


def _keyword(call, name):
    for kw in call.keywords:
        if kw.arg == name:
            return kw
    return None


class RecordPathRule(Rule):
    """``lock.record-path``: declared record-path functions may not
    acquire locks, block, do I/O, or force a device sync — the
    flight-recorder discipline (PR 10's overhead contract: a stage
    mark is one enabled-flag check + one GIL-atomic container op)."""

    id = "lock.record-path"
    family = "lock"
    doc = ("record-path functions must stay lock-free, I/O-free and "
           "device-sync-free")

    def check_file(self, path, tree, lines):
        declared = self.registry.record_path_functions(path)
        if declared == ():
            return
        quals = _qualify(tree)
        for node, qual in quals.items():
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if declared is not None and qual not in declared:
                continue
            # whole-module declarations visit every def under its OWN
            # qualname, so each checks only its own scope (a nested
            # violation must not be reported twice); an explicitly
            # declared function also owns its nested closures — they
            # are not separately declared
            yield from self._check_function(
                path, node, qual, include_nested=declared is not None)

    def _check_function(self, path, func, qual, include_nested=False):
        nodes = list(_walk_scope(func))
        if include_nested:
            for child in ast.walk(func):
                if child is not func \
                        and isinstance(child, (ast.FunctionDef,
                                               ast.AsyncFunctionDef)):
                    nodes.extend(_walk_scope(child))
        for node in nodes:
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if _is_lockish(item.context_expr):
                        yield Finding(
                            self.id, path, item.context_expr.lineno,
                            "record-path function %s acquires a lock "
                            "(%s) — the flight-recorder discipline "
                            "allows GIL-atomic container ops only"
                            % (qual,
                               _dotted(item.context_expr) or "with"))
            elif isinstance(node, ast.Call):
                yield from self._check_call(path, node, qual)

    def _check_call(self, path, call, qual):
        func = call.func
        if isinstance(func, ast.Name) \
                and func.id in _RECORD_PATH_BANNED_NAMES:
            yield Finding(
                self.id, path, call.lineno,
                "record-path function %s calls %s() — I/O is forbidden "
                "on the record path" % (qual, func.id))
            return
        if isinstance(func, ast.Attribute):
            if func.attr == "acquire":
                yield Finding(
                    self.id, path, call.lineno,
                    "record-path function %s calls .acquire() — the "
                    "record path must stay lock-free" % qual)
                return
            if func.attr in _DEVICE_SYNC_METHODS:
                yield Finding(
                    self.id, path, call.lineno,
                    "record-path function %s calls .%s() — a device "
                    "sync stalls every thread behind the dispatch"
                    % (qual, func.attr))
                return
            if func.attr in _LOGGING_METHODS \
                    and isinstance(func.value, ast.Name) \
                    and func.value.id in ("self", "logger", "log",
                                          "logging"):
                yield Finding(
                    self.id, path, call.lineno,
                    "record-path function %s logs via .%s() — logging "
                    "handlers flush to streams/files; record, don't "
                    "narrate" % (qual, func.attr))
                return
            dotted = _dotted(func)
            if dotted:
                key = tuple(dotted.split(".")[-2:])
                why = _RECORD_PATH_BANNED_ATTRS.get(key)
                if why:
                    yield Finding(
                        self.id, path, call.lineno,
                        "record-path function %s calls %s — %s"
                        % (qual, dotted, why))


class LockOrderingRule(Rule):
    """``lock.ordering``: within one class, two methods must not nest
    the same pair of lock attributes in opposite orders — the classic
    deadlock-by-inversion (each inverted edge is reported where the
    second ordering appears)."""

    id = "lock.ordering"
    family = "lock"
    doc = "lock-acquisition nesting across a class must be acyclic"

    def check_file(self, path, tree, lines):
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(path, node)

    def _check_class(self, path, cls):
        edges = {}  # (outer, inner) -> (method, line)

        def walk(node, held, method):
            stack = list(held)
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    name = self._lock_name(item.context_expr)
                    if name:
                        for outer in stack:
                            edge = (outer, name)
                            edges.setdefault(
                                edge, (method, item.context_expr.lineno))
                        stack.append(name)
            for child in ast.iter_child_nodes(node):
                walk(child, stack, method)

        for item in cls.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                walk(item, [], item.name)
        for (outer, inner), (method, line) in sorted(
                edges.items(), key=lambda kv: kv[1][1]):
            if (inner, outer) in edges and outer < inner:
                other_method, other_line = edges[(inner, outer)]
                report = max((method, line), (other_method, other_line),
                             key=lambda pair: pair[1])
                yield Finding(
                    self.id, path, report[1],
                    "class %s acquires %s->%s in %s (line %d) but "
                    "%s->%s in %s (line %d) — lock-order inversion"
                    % (cls.name, outer, inner, method, line,
                       inner, outer, other_method, other_line))

    @staticmethod
    def _lock_name(expr):
        if isinstance(expr, ast.Attribute) \
                and LOCK_ATTR_RE.search(expr.attr):
            return _dotted(expr) or expr.attr
        if isinstance(expr, ast.Name) and LOCK_ATTR_RE.search(expr.id):
            return expr.id
        return None


class UnpinnedOutShardingsRule(Rule):
    """``retrace.unpinned-out-shardings``: a ``jax.jit`` call that pins
    ``in_shardings`` (a mesh-layout program) must pin ``out_shardings``
    too — otherwise a donated state adopts whatever layout the last
    program preferred and every admit retraces (the PR 6 storm)."""

    id = "retrace.unpinned-out-shardings"
    family = "retrace"
    doc = "mesh-jitted programs must pin out_shardings"

    def check_file(self, path, tree, lines):
        for node in ast.walk(tree):
            if not _is_jit_call(node):
                continue
            if _keyword(node, "in_shardings") is not None \
                    and _keyword(node, "out_shardings") is None:
                yield Finding(
                    self.id, path, node.lineno,
                    "jax.jit call pins in_shardings but not "
                    "out_shardings — the output layout floats and "
                    "donated state drifts into retrace storms "
                    "(pin it like decode.slot_fns)")


def _walk_scope(node):
    """Walk a function's OWN statements — never descending into nested
    function/class defs (those run in a different dynamic scope)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef, ast.Lambda)):
            continue
        yield child
        yield from _walk_scope(child)


class LocalJitDispatchRule(Rule):
    """``retrace.local-jit-dispatch``: building a jit around a
    PER-CALL callable (a local def of this very function, a lambda, or
    a fresh ``shard_map(...)`` wrapper) and dispatching it in the same
    scope — the jit cache keys on the callable's identity, and a fresh
    object is born per enclosing call, so EVERY call re-traces (the
    compile counters read it as a permanent storm). Builders that jit
    once and RETURN the result (the caller holds one object) are
    exempt, as is jitting a module-level function (stable identity)."""

    id = "retrace.local-jit-dispatch"
    family = "retrace"
    doc = "jit of a per-call callable dispatched in the same scope"

    def check_file(self, path, tree, lines):
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_function(path, node)

    def _check_function(self, path, func):
        local_defs = {child.name for child in func.body
                      if isinstance(child, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))}
        jitted = {}  # bound name -> (jit line, wrapped description)
        for stmt in _walk_scope(func):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and _is_jit_call(stmt.value):
                wrapped = self._per_call_identity(stmt.value,
                                                  local_defs)
                if wrapped:
                    jitted[stmt.targets[0].id] = (stmt.value.lineno,
                                                  wrapped)
        if not jitted:
            return
        # two sanctioned memo shapes survive across calls and carry no
        # per-call identity: a jit stored into a keyed cache
        # (`fn = jax.jit(...)` guarded by `_FN_CACHE.get(key)` then
        # `_FN_CACHE[key] = fn`), and a jit assigned to a nonlocal/
        # global closure slot BEHIND a guard that mentions the slot
        # (`nonlocal tp_fn; if tp_fn is None: tp_fn = ...`) — an
        # UNGUARDED nonlocal rebuild still re-traces every call
        guarded = self._guard_tested_names(func)
        memo_names = set()
        for stmt in _walk_scope(func):
            if isinstance(stmt, (ast.Nonlocal, ast.Global)):
                memo_names.update(stmt.names)
        for stmt in _walk_scope(func):
            if isinstance(stmt, ast.Assign) \
                    and any(isinstance(t, ast.Subscript)
                            for t in stmt.targets) \
                    and isinstance(stmt.value, ast.Name):
                jitted.pop(stmt.value.id, None)
        for name in memo_names & guarded:
            jitted.pop(name, None)
        for node in _walk_scope(func):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in jitted:
                line, wrapped = jitted[node.func.id]
                yield Finding(
                    self.id, path, node.lineno,
                    "dispatching %r, a jit (line %d) of %s — a fresh "
                    "callable identity per %s() call means EVERY call "
                    "re-traces; hoist the jit to module scope or "
                    "cache it keyed on its statics"
                    % (node.func.id, line, wrapped, func.name))

    @staticmethod
    def _guard_tested_names(func):
        """Names assigned inside an ``if`` whose test mentions them —
        the `if slot is None: slot = ...` memo-guard shape."""
        guarded = set()

        def visit(node, tests):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.ClassDef, ast.Lambda)):
                    continue
                child_tests = tests
                if isinstance(node, ast.If) and child in node.body:
                    child_tests = tests | {
                        n.id for n in ast.walk(node.test)
                        if isinstance(n, ast.Name)}
                if isinstance(child, ast.Assign):
                    for target in child.targets:
                        if isinstance(target, ast.Name) \
                                and target.id in child_tests:
                            guarded.add(target.id)
                visit(child, child_tests)

        visit(func, frozenset())
        return guarded

    @staticmethod
    def _per_call_identity(jit_call, local_defs):
        """A description of the per-call-identity callable this jit
        wraps, or None when the wrapped object is identity-stable."""
        if not jit_call.args:
            return None
        target = jit_call.args[0]
        if isinstance(target, ast.Lambda):
            return "a lambda"
        if isinstance(target, ast.Call):
            dotted = _dotted(target.func)
            if dotted and dotted.split(".")[-1] == "shard_map":
                return "a fresh shard_map wrapper"
            return None
        if isinstance(target, ast.Name) and target.id in local_defs:
            return "local def %r" % target.id
        return None


class UnhashableStaticRule(Rule):
    """``retrace.unhashable-static``: passing a list/dict/set literal
    for a declared ``static_argnames`` parameter of a module-local jit
    wrapper — statics key the jit cache, an unhashable one raises and a
    call-varying one retraces per call."""

    id = "retrace.unhashable-static"
    family = "retrace"
    doc = "jit statics must be hashable, canonical values"

    _MUTABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                ast.SetComp)

    def check_file(self, path, tree, lines):
        statics = {}  # local name -> set of static argnames
        for node in ast.walk(tree):
            target = None
            call = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and _is_jit_call(node.value):
                target, call = node.targets[0].id, node.value
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) \
                            and _dotted(dec.func) == "functools.partial" \
                            and dec.args and _dotted(dec.args[0]) in (
                                "jax.jit", "jit"):
                        target, call = node.name, dec
            if call is None:
                continue
            kw = _keyword(call, "static_argnames")
            names = self._literal_strings(kw.value) if kw else set()
            if names:
                statics[target] = names
        if not statics:
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Name):
                continue
            names = statics.get(node.func.id)
            if not names:
                continue
            for kw in node.keywords:
                if kw.arg in names \
                        and isinstance(kw.value, self._MUTABLE):
                    yield Finding(
                        self.id, path, kw.value.lineno,
                        "call passes a mutable %s for static arg %r of "
                        "jitted %s — statics must be hashable (use a "
                        "tuple) or the dispatch raises/retraces"
                        % (type(kw.value).__name__.lower(), kw.arg,
                           node.func.id))

    @staticmethod
    def _literal_strings(node):
        out = set()
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            for element in node.elts:
                if isinstance(element, ast.Constant) \
                        and isinstance(element.value, str):
                    out.add(element.value)
        elif isinstance(node, ast.Constant) \
                and isinstance(node.value, str):
            out.add(node.value)
        return out


class JitInLoopRule(Rule):
    """``retrace.jit-in-loop``: constructing ``jax.jit(...)`` inside a
    loop body builds a FRESH traced callable per iteration — nothing is
    cached across iterations, so every pass pays a retrace. Filling a
    keyed cache (``cache[key] = jax.jit(...)`` / ``setdefault``) is the
    sanctioned shape and is exempt."""

    id = "retrace.jit-in-loop"
    family = "retrace"
    doc = "jit construction inside a loop retraces per iteration"

    def check_file(self, path, tree, lines):
        findings = []
        scopes = [tree] + [n for n in ast.walk(tree)
                           if isinstance(n, (ast.FunctionDef,
                                             ast.AsyncFunctionDef))]
        for scope in scopes:
            findings.extend(self._check_scope(path, scope))
        return findings

    def _check_scope(self, path, scope):
        # names that flow into a keyed cache IN THIS SCOPE
        # (`cache[k] = fn`, `cache.setdefault(k, fn)`): the miss-branch
        # shape builds the jit in the loop but caches it — no
        # per-iteration retrace. Scope-local so an unrelated
        # function's `cache[k] = fn` cannot silence a same-named
        # uncached jit elsewhere in the file.
        cached_names = set()
        for node in _walk_scope(scope):
            if isinstance(node, ast.Assign) \
                    and any(isinstance(t, ast.Subscript)
                            for t in node.targets) \
                    and isinstance(node.value, ast.Name):
                cached_names.add(node.value.id)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "setdefault":
                cached_names.update(a.id for a in node.args
                                    if isinstance(a, ast.Name))
        findings = []

        def visit(node, in_loop, stmt):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.ClassDef, ast.Lambda)):
                    continue  # a separate scope (checked on its own)
                child_in_loop = in_loop
                if isinstance(node, (ast.For, ast.While)) \
                        and child in getattr(node, "body", ()):
                    child_in_loop = True
                child_stmt = child if isinstance(child, ast.stmt) \
                    else stmt
                if child_in_loop and _is_jit_call(child) \
                        and not self._fills_cache(child_stmt,
                                                  cached_names):
                    findings.append(Finding(
                        self.id, path, child.lineno,
                        "jax.jit constructed inside a loop — a fresh "
                        "traced callable per iteration, nothing cached; "
                        "hoist it or store it in a keyed cache"))
                visit(child, child_in_loop, child_stmt)

        visit(scope, False, None)
        return findings

    @staticmethod
    def _fills_cache(stmt, cached_names):
        if stmt is None:
            return False
        if isinstance(stmt, ast.Assign):
            if any(isinstance(t, ast.Subscript) for t in stmt.targets):
                return True
            # the miss-branch shape: `fn = jax.jit(...)` whose name is
            # stored into a keyed cache elsewhere in the file
            return any(isinstance(t, ast.Name) and t.id in cached_names
                       for t in stmt.targets)
        if isinstance(stmt, ast.Expr) \
                and isinstance(stmt.value, ast.Call) \
                and isinstance(stmt.value.func, ast.Attribute):
            return stmt.value.func.attr == "setdefault"
        return False


class ShapeKeyRule(Rule):
    """``retrace.shape-key``: program/shape caches must key on
    canonical hashable tuples — a list/set/dict (or ``list(...)`` /
    ``set(...)`` call) in the key raises at runtime or, worse, keys on
    identity and silently re-traces per call."""

    id = "retrace.shape-key"
    family = "retrace"
    doc = "shape caches must key on canonical tuples"

    _CACHEY = re.compile(r"cache|_fns|programs|jit", re.IGNORECASE)
    _BAD = (ast.List, ast.Set, ast.Dict, ast.ListComp, ast.SetComp)

    def check_file(self, path, tree, lines):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            for target in node.targets:
                if not isinstance(target, ast.Subscript):
                    continue
                container = _dotted(target.value) or ""
                if not self._CACHEY.search(container):
                    continue
                bad = self._bad_key(target.slice)
                if bad is not None:
                    yield Finding(
                        self.id, path, node.lineno,
                        "%s is keyed on a non-canonical %s — shape "
                        "keys must be hashable tuples (one compiled "
                        "program per canonical key is the "
                        "dispatch-economy invariant)"
                        % (container, bad))

    def _bad_key(self, key):
        for node in ast.walk(key):
            if isinstance(node, self._BAD):
                return type(node).__name__.lower()
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in ("list", "set", "dict"):
                return "%s(...) call" % node.func.id
        return None


class DonationReadAfterDispatchRule(Rule):
    """``donation.read-after-dispatch``: an argument at a donated
    position is DEAD once the jitted call returns — XLA may already
    have reused its buffer (PR 9's doctrine). Reading the same name
    later in the same straight-line scope (before rebinding) is flagged."""

    id = "donation.read-after-dispatch"
    family = "donation"
    doc = "donated buffers must not be read after the jitted call"

    def check_file(self, path, tree, lines):
        donated = self._collect_donated(tree)
        scopes = [tree] + [n for n in ast.walk(tree)
                           if isinstance(n, (ast.FunctionDef,
                                             ast.AsyncFunctionDef))]
        for scope in scopes:
            yield from self._check_body(path, scope.body, donated)

    @staticmethod
    def _collect_donated(tree):
        """Local names bound to jit wrappers with donate_argnums →
        donated positional indices."""
        donated = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign) \
                    or len(node.targets) != 1 \
                    or not isinstance(node.targets[0], ast.Name) \
                    or not _is_jit_call(node.value):
                continue
            kw = _keyword(node.value, "donate_argnums")
            if kw is None:
                continue
            indices = []
            if isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, int):
                indices = [kw.value.value]
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                indices = [e.value for e in kw.value.elts
                           if isinstance(e, ast.Constant)
                           and isinstance(e.value, int)]
            if indices:
                donated[node.targets[0].id] = tuple(indices)
        return donated

    def _check_body(self, path, body, donated):
        """Straight-line scan of one statement list: after a call that
        donates name N, a Load of N before a rebinding is a finding."""
        dead = {}  # name -> (call line, callee)
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            # reads first: the canonical `state = step(state)` rebind
            # reads the pre-call value, which is fine
            for node in ast.walk(stmt):
                if isinstance(node, ast.Name) \
                        and isinstance(node.ctx, ast.Load) \
                        and node.id in dead:
                    line, callee = dead[node.id]
                    yield Finding(
                        self.id, path, node.lineno,
                        "%r is read after being donated to %s (line "
                        "%d) — the buffer may already be reused; "
                        "copy before the call or use the returned "
                        "value" % (node.id, callee, line))
                    dead.pop(node.id, None)
            # then rebindings revive names
            stored = set()
            for node in ast.walk(stmt):
                if isinstance(node, ast.Name) \
                        and isinstance(node.ctx, (ast.Store, ast.Del)):
                    dead.pop(node.id, None)
                    stored.add(node.id)
            # then this statement's donations take effect — but a name
            # REBOUND by the same statement (`state = step(state, b)`)
            # now holds the returned value, not the donated buffer
            donated_uses = {}  # name -> donated-position use count
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name) \
                        and node.func.id in donated:
                    for index in donated[node.func.id]:
                        if index < len(node.args):
                            arg = node.args[index]
                            if isinstance(arg, ast.Name) \
                                    and arg.id not in stored:
                                dead[arg.id] = (node.lineno,
                                                node.func.id)
                                donated_uses[arg.id] = \
                                    donated_uses.get(arg.id, 0) + 1
            # a SAME-statement read beyond the donated-arg position
            # (`return step(state, b) + state`) already reads the
            # possibly-reused buffer
            for name, uses in donated_uses.items():
                loads = sum(1 for n in ast.walk(stmt)
                            if isinstance(n, ast.Name)
                            and isinstance(n.ctx, ast.Load)
                            and n.id == name)
                if loads > uses:
                    line, callee = dead[name]
                    yield Finding(
                        self.id, path, stmt.lineno,
                        "%r is read in the same statement that "
                        "donates it to %s — the buffer may already "
                        "be reused; copy before the call or use the "
                        "returned value" % (name, callee))

class SharedRmwRule(Rule):
    """``shared.rmw``: on declared handler+driver shared classes, an
    attribute read-modify-write (``self.x += 1``,
    ``self.d[k] = self.d.get(k, 0) + 1``) is NOT GIL-atomic — two
    threads interleave load/op/store and drop updates. Such mutations
    must run under the class's lock (``with self._lock:``)."""

    id = "shared.rmw"
    family = "shared-state"
    doc = ("read-modify-write on shared classes must hold the class "
           "lock")

    def check_file(self, path, tree, lines):
        declared = self.registry.shared_classes_for(path)
        if not declared:
            return
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and node.name in declared:
                exempt = set(declared[node.name]) | {"__init__"}
                yield from self._check_class(path, node, exempt)

    def _check_class(self, path, cls, exempt):
        for item in cls.body:
            if not isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                    or item.name in exempt:
                continue
            yield from self._check_method(path, cls.name, item)

    def _check_method(self, path, cls_name, method):
        findings = []

        def visit(node, locked):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                if any(_is_lockish(i.context_expr) for i in node.items):
                    locked = True
            if not locked:
                rmw = self._rmw(node)
                if rmw:
                    findings.append(Finding(
                        self.id, path, node.lineno,
                        "%s.%s mutates %s outside the class lock — "
                        "load/op/store interleaves across threads and "
                        "drops updates (wrap in `with self.<lock>:`)"
                        % (cls_name, method.name, rmw)))
            for child in ast.iter_child_nodes(node):
                visit(child, locked)

        visit(method, False)
        return findings

    @staticmethod
    def _self_attr(node):
        """``self.x`` or ``self.x[...]`` → dotted description."""
        if isinstance(node, ast.Subscript):
            node = node.value
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            return "self." + node.attr
        return None

    def _rmw(self, node):
        if isinstance(node, ast.AugAssign):
            return self._self_attr(node.target)
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = self._self_attr(node.targets[0])
            if target is None:
                return None
            # self.d[k] = ... self.d.get(...) / self.d[...] ... is a
            # two-step read-modify-write on the same attribute
            for sub in ast.walk(node.value):
                if self._self_attr(sub) == target \
                        and isinstance(sub, (ast.Subscript,
                                             ast.Attribute)) \
                        and sub is not node.targets[0]:
                    return target
        return None


# -- zero-downtime deploys (ISSUE 16's drain-seam doctrine) ----------------

#: the live-weight attributes a serving engine exposes
_WEIGHT_ATTRS = {"params", "embed_table"}
#: the only methods sanctioned to write them on ``self``: the
#: constructor (no concurrency before publication) and the drain-seam
#: swap itself
_SEAM_METHODS = {"__init__", "swap_params"}


class SwapSeamRule(Rule):
    """``deploy.swap-seam``: live weights (``.params`` /
    ``.embed_table``) may only be written inside the drain seam. The
    serving drive loop reads them on every dispatch; a handler thread
    (or governor callback) assigning ``decoder.params = new`` races
    requests mid-decode onto half-swapped weights. The sanctioned
    writers are ``__init__`` (no concurrency before publication) and
    the object's own ``swap_params`` — which the drive loop invokes
    via ``request_swap`` only once both engines are drained. Reaching
    through another object (``self.decoder.params = ...``) is never
    sanctioned: route it through ``request_swap()``."""

    id = "deploy.swap-seam"
    family = "deploy"
    doc = ("live weights may only be written at the drain seam "
           "(__init__/swap_params on self; request_swap otherwise)")

    def check_file(self, path, tree, lines):
        findings = []

        def visit(node, fn_name):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                fn_name = node.name
            for target in self._write_targets(node):
                findings.append(self._judge(path, target, fn_name))
            for child in ast.iter_child_nodes(node):
                visit(child, fn_name)

        visit(tree, None)
        return [f for f in findings if f is not None]

    @staticmethod
    def _write_targets(node):
        """Attribute targets of assignments to a weight attribute."""
        if isinstance(node, ast.AugAssign):
            targets = [node.target]
        elif isinstance(node, ast.Assign):
            targets = []
            for t in node.targets:
                targets.extend(t.elts if isinstance(t, ast.Tuple)
                               else [t])
        else:
            return ()
        return [t for t in targets
                if isinstance(t, ast.Attribute)
                and t.attr in _WEIGHT_ATTRS]

    def _judge(self, path, target, fn_name):
        owner = target.value
        on_self = isinstance(owner, ast.Name) and owner.id == "self"
        if on_self and fn_name in _SEAM_METHODS:
            return None
        dotted = _dotted(target) or target.attr
        if on_self:
            detail = ("an engine may only rebind its own weights in "
                      "__init__ or swap_params")
        else:
            detail = ("reaching into another object's live weights "
                      "races the drive loop mid-dispatch — call "
                      "request_swap() so the swap lands at the "
                      "drained seam")
        return Finding(
            self.id, path, target.lineno,
            "write to %s outside the drain seam — %s"
            % (dotted, detail))


# -- metric hygiene (PR 5's grammar, promoted from the test suite) ---------

#: stricter than METRIC_NAME_RE: the repo convention is lowercase
#: veles_-prefixed tokens (the runtime grammar also allows colons and
#: uppercase, which scrapers accept but this codebase bans)
_METRIC_TOKEN_RE = re.compile(r"^veles_[a-z][a-z0-9_]*$")
_COUNTER_METHODS = {"incr", "counter_set"}
_HISTOGRAM_METHODS = {"observe"}
_GAUGE_METHODS = {"set", "set_gauge_family"}
_METRIC_METHODS = (_COUNTER_METHODS | _HISTOGRAM_METHODS
                   | _GAUGE_METHODS)


def iter_metric_calls(tree):
    """Every registry-method call with a literal ``veles_*`` name:
    ``(node, method, name, label_keys, has_help)`` rows — shared by
    both metric rules and by the test-suite wrapper."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute):
            continue
        method = node.func.attr
        if method not in _METRIC_METHODS:
            continue
        if not node.args or not isinstance(node.args[0], ast.Constant) \
                or not isinstance(node.args[0].value, str):
            continue
        name = node.args[0].value
        if not name.startswith("veles_"):
            continue
        labels = []
        has_help = False
        for kw in node.keywords:
            if kw.arg == "labels" and isinstance(kw.value, ast.Dict):
                for key in kw.value.keys:
                    if isinstance(key, ast.Constant):
                        labels.append(key.value)
            if kw.arg == "help" and not (
                    isinstance(kw.value, ast.Constant)
                    and kw.value.value in (None, "")):
                has_help = True
        yield node, method, name, labels, has_help


class MetricNamingRule(Rule):
    """``metric.naming``: every literal ``veles_*`` metric must be a
    lowercase exposition token; counters end ``_total``, histograms end
    ``_seconds``, gauges carry neither suffix; label keys are valid and
    never the reserved ``le`` or ``__``-prefixed."""

    id = "metric.naming"
    family = "metric"
    doc = "veles_* metrics must follow the Prometheus grammar"

    def check_file(self, path, tree, lines):
        for node, method, name, labels, _ in iter_metric_calls(tree):
            where = node.lineno
            if not METRIC_NAME_RE.match(name) \
                    or not _METRIC_TOKEN_RE.match(name):
                yield Finding(
                    self.id, path, where,
                    "%r is not a valid lowercase veles_* metric token"
                    % name)
            if method in _COUNTER_METHODS \
                    and not name.endswith("_total"):
                yield Finding(
                    self.id, path, where,
                    "counter %r must end _total" % name)
            if method in _HISTOGRAM_METHODS \
                    and not name.endswith("_seconds"):
                yield Finding(
                    self.id, path, where,
                    "histogram %r must end _seconds" % name)
            if method in _GAUGE_METHODS \
                    and name.endswith(("_total", "_seconds")):
                yield Finding(
                    self.id, path, where,
                    "gauge %r carries a counter/histogram suffix"
                    % name)
            for label in labels:
                if not isinstance(label, str) \
                        or not LABEL_NAME_RE.match(label) \
                        or label == "le" or label.startswith("__"):
                    yield Finding(
                        self.id, path, where,
                        "bad label key %r on %r (reserved or invalid "
                        "exposition token)" % (label, name))


class MetricHelpRule(Rule):
    """``metric.help``: every metric FAMILY must carry a HELP string at
    (at least) one call site — a family whose every booking omits
    ``help=`` renders a bare ``# HELP`` line dashboards cannot
    explain. Cross-file: reported at the family's first call site.
    WHOLE-PACKAGE rule — on a partial-path run a family's help may
    legitimately live in an unanalyzed file; the CI gate always runs
    the full tree."""

    id = "metric.help"
    family = "metric"
    doc = "every veles_* family needs a HELP string somewhere"

    def configure(self, registry):
        super().configure(registry)
        self._first_site = {}   # name -> (path, line)
        self._has_help = set()

    def check_file(self, path, tree, lines):
        for node, _, name, _, has_help in iter_metric_calls(tree):
            if has_help:
                self._has_help.add(name)
            self._first_site.setdefault(name, (path, node.lineno))
        return ()

    def finalize(self):
        for name, (path, line) in sorted(self._first_site.items()):
            if name not in self._has_help:
                yield Finding(
                    self.id, path, line,
                    "metric family %r never passes help= at any call "
                    "site — add a HELP string at one booking site"
                    % name)


def default_rules():
    """Fresh instances of every shipped rule (order = catalog order)."""
    return [RecordPathRule(), LockOrderingRule(),
            UnpinnedOutShardingsRule(), LocalJitDispatchRule(),
            UnhashableStaticRule(), JitInLoopRule(), ShapeKeyRule(),
            DonationReadAfterDispatchRule(), SharedRmwRule(),
            SwapSeamRule(), MetricNamingRule(), MetricHelpRule()]
