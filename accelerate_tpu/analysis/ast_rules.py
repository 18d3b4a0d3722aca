"""AST rule engine: the fast repo-wide half of graft-lint.

Where the jaxpr auditor sees the traced program, this engine sees the
source — the two halves cover each other's blind spots.  Caller-side
donated-buffer reuse (GL201, the PR 2 async-checkpoint race shape) happens
*after* the jitted call returns, so no jaxpr contains it; ``time.time()``
inside a jitted function (GL204) leaves no trace at all — the trace bakes
the first call's value silently.

**Jit contexts.**  GL202/GL204 only fire inside code that runs under trace.
A function is a jit context when it (a) is decorated with ``jax.jit`` /
``jax.pmap`` (bare, called, or via ``partial``), (b) is passed by name to a
``jax.jit(...)`` call anywhere in the module, (c) is lexically nested
inside a jit context, or (d) is called by bare name from inside one (the
call graph is closed transitively — ``pinned_step_fn -> step_fn ->
compute_grads`` in the accelerator all count).

**Donated-reuse (GL201).**  The engine records every ``name = jax.jit(fn,
donate_argnums=...)`` binding in the module, then at each call of such a
name treats the bare-``Name`` arguments in donated positions as dead: a
later *load* of that name in the same scope is a finding, unless a
rebinding (``state, m = jitted(state, batch)``) or ``del`` intervenes.
Known miss (documented in docs/static_analysis.md): reuse across loop
iterations with no textual load after the call line.

Suppression: the shared inline marker (see :mod:`.report`) on the flagged
line or the line above.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable, Optional, Sequence

from .report import Finding, Report, apply_suppressions, parse_marker
from .rules import RULES

# path substrings every repo-wide run skips: intentionally-buggy lint fixtures
DEFAULT_EXCLUDES = ("tests/analysis_fixtures",)

# directory names pruned from directory sweeps (matched as whole path
# components, so `venv/` is skipped but `myvenv_utils.py` is not):
# vendored/generated trees whose findings are never actionable here
DEFAULT_EXCLUDE_DIRS = frozenset({
    "__pycache__", ".git", ".venv", "venv", ".eggs", ".tox", "build",
    "dist", "node_modules", "site-packages",
})

_HOST_SYNC_METHODS = frozenset({"item", "tolist"})
_HOST_SYNC_NP_FUNCS = frozenset({"asarray", "array"})
_IMPURE_TIME_FUNCS = frozenset({"time", "perf_counter", "monotonic", "time_ns", "process_time"})


def _finding(rule_id: str, message: str, path: str, line: int) -> Finding:
    r = RULES[rule_id]
    return Finding(
        rule=rule_id, severity=r.severity, message=message, fix_hint=r.fix_hint,
        path=path, line=line, engine="ast",
    )


def _dotted(node) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _ModuleIndex:
    """One pass of bookkeeping the rules share: import aliases, function
    defs with nesting, jit-context closure, donated-jit bindings."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        # local name -> canonical dotted name ("np" -> "numpy")
        self.aliases: dict[str, str] = {}
        self.functions: list[ast.FunctionDef] = []
        self._parent: dict[int, Optional[ast.AST]] = {}
        # function name -> donated positional indices, for jax.jit bindings
        self.donated_callables: dict[str, tuple[int, ...]] = {}
        # every name bound to a jax.jit/jax.pmap wrapper (donating or not) —
        # calls of these are "jitted calls" for the timing rule (GL109)
        self.jit_bound_names: set[str] = set()
        self._index()
        self.jit_contexts = self._close_jit_contexts()

    # -- construction ------------------------------------------------------

    def _index(self):
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parent[id(child)] = node
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.aliases[a.asname or a.name] = f"{node.module}.{a.name}"
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions.append(node)
            elif isinstance(node, ast.Assign) or isinstance(node, ast.AnnAssign):
                self._record_donated_binding(node)

    def canonical(self, node) -> Optional[str]:
        """Dotted name with the leading import alias resolved."""
        dotted = _dotted(node)
        if dotted is None:
            return None
        head, _, rest = dotted.partition(".")
        head = self.aliases.get(head, head)
        return f"{head}.{rest}" if rest else head

    def _is_jit_call(self, node) -> bool:
        return (
            isinstance(node, ast.Call)
            and self.canonical(node.func) in ("jax.jit", "jax.pmap")
        )

    def _record_donated_binding(self, assign):
        targets = assign.targets if isinstance(assign, ast.Assign) else [assign.target]
        value = assign.value
        if not (self._is_jit_call(value) and len(targets) == 1
                and isinstance(targets[0], ast.Name)):
            return
        self.jit_bound_names.add(targets[0].id)
        donated = _donate_positions(value)
        if donated:
            self.donated_callables[targets[0].id] = donated

    # -- jit-context closure ----------------------------------------------

    def enclosing_function(self, node) -> Optional[ast.AST]:
        cur = self._parent.get(id(node))
        while cur is not None and not isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cur = self._parent.get(id(cur))
        return cur

    def _decorated_as_jit(self, fn) -> bool:
        for dec in fn.decorator_list:
            target = dec
            if isinstance(dec, ast.Call):
                # @jax.jit(...) or @partial(jax.jit, ...)
                if self.canonical(dec.func) in ("jax.jit", "jax.pmap"):
                    return True
                if (self.canonical(dec.func) in ("functools.partial", "partial")
                        and dec.args
                        and self.canonical(dec.args[0]) in ("jax.jit", "jax.pmap")):
                    return True
                continue
            if self.canonical(target) in ("jax.jit", "jax.pmap"):
                return True
        return False

    def _close_jit_contexts(self) -> set:
        by_name: dict[str, list] = {}
        for fn in self.functions:
            by_name.setdefault(fn.name, []).append(fn)
        seeds: set = set()
        for fn in self.functions:
            if self._decorated_as_jit(fn):
                seeds.add(id(fn))
        # functions passed by name into jax.jit(...)
        for node in ast.walk(self.tree):
            if self._is_jit_call(node) and node.args:
                name = _dotted(node.args[0])
                for fn in by_name.get(name or "", []):
                    seeds.add(id(fn))
        # transitive closure over lexical nesting + bare-name calls
        contexts = set(seeds)
        changed = True
        while changed:
            changed = False
            for fn in self.functions:
                if id(fn) in contexts:
                    continue
                parent = self.enclosing_function(fn)
                if parent is not None and id(parent) in contexts:
                    contexts.add(id(fn))
                    changed = True
            for fn in self.functions:
                if id(fn) not in contexts:
                    continue
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                        for callee in by_name.get(node.func.id, []):
                            if id(callee) not in contexts:
                                contexts.add(id(callee))
                                changed = True
        return contexts

    def in_jit_context(self, node) -> bool:
        fn = self.enclosing_function(node)
        return fn is not None and id(fn) in self.jit_contexts


def _donate_positions(jit_call: ast.Call) -> tuple[int, ...]:
    """Literal donate_argnums of a jax.jit(...) call; a non-literal value
    conservatively reads as ``(0,)`` (the overwhelmingly common case —
    the accelerator's ``donate_argnums=(0,) if donate_state else ()``)."""
    for kw in jit_call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            if not v.elts:
                return ()  # explicit empty literal: donates nothing
            out = tuple(
                e.value for e in v.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)
            )
            return out or (0,)
        return (0,)
    return ()


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------


def _rule_donated_reuse(index: _ModuleIndex, path: str) -> list[Finding]:
    """GL201: a donated name loaded after the donating call in its scope."""
    findings = []
    scopes: list = [index.tree] + list(index.functions)
    for scope in scopes:
        own = (
            lambda n: index.enclosing_function(n) is scope
            if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef))
            else index.enclosing_function(n) is None
        )
        calls = []  # (call node, donated arg names)
        for node in ast.walk(scope):
            if not (isinstance(node, ast.Call) and own(node)):
                continue
            donated: tuple[int, ...] = ()
            if isinstance(node.func, ast.Name) and node.func.id in index.donated_callables:
                donated = index.donated_callables[node.func.id]
            elif isinstance(node.func, ast.Call) and index._is_jit_call(node.func):
                donated = _donate_positions(node.func)  # jax.jit(f, ...)(x)
            names = [
                node.args[i].id
                for i in donated
                if i < len(node.args) and isinstance(node.args[i], ast.Name)
            ]
            if names:
                calls.append((node, names))
        if not calls:
            continue
        name_events: dict[str, list] = {}
        for node in ast.walk(scope):
            if isinstance(node, ast.Name) and own(node):
                name_events.setdefault(node.id, []).append(node)
        for call, names in calls:
            call_end = getattr(call, "end_lineno", call.lineno) or call.lineno
            for name in names:
                for ev in sorted(name_events.get(name, []),
                                 key=lambda n: (n.lineno, n.col_offset)):
                    if ev.lineno < call.lineno:
                        continue
                    aug = isinstance(index._parent.get(id(ev)), ast.AugAssign)
                    if isinstance(ev.ctx, (ast.Store, ast.Del)) and not aug:
                        # rebound/deleted at or after the call (the canonical
                        # `state, m = jitted(state, b)`): the donated buffer
                        # is dead under this name.  An AugAssign target is
                        # NOT safe — `state += 1` reads the donated buffer
                        # before writing it.
                        break
                    if not aug and ev.lineno <= call_end:
                        continue  # the call's own argument load
                    findings.append(
                        _finding(
                            "GL201",
                            f"`{name}` was donated at line {call.lineno} "
                            "(donate_argnums) but is read again here — its "
                            "buffer may already be overwritten in place",
                            path, ev.lineno,
                        )
                    )
                    break
    return findings


def _rule_host_sync(index: _ModuleIndex, path: str) -> list[Finding]:
    """GL202: host-synchronizing calls inside jit contexts."""
    findings = []
    for node in ast.walk(index.tree):
        if not (isinstance(node, ast.Call) and index.in_jit_context(node)):
            continue
        msg = None
        if isinstance(node.func, ast.Attribute) and node.func.attr in _HOST_SYNC_METHODS:
            msg = f".{node.func.attr}() forces a device->host sync"
        else:
            canon = index.canonical(node.func)
            if canon in {f"numpy.{f}" for f in _HOST_SYNC_NP_FUNCS}:
                msg = f"{canon}() materializes a traced value on host"
            elif canon in ("float", "int", "bool") and node.args:
                arg = node.args[0]
                fn = index.enclosing_function(node)
                params = set()
                if fn is not None:
                    a = fn.args
                    params = {p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)}
                if isinstance(arg, ast.Name) and arg.id in params:
                    msg = f"{canon}() on traced argument `{arg.id}` concretizes it"
        if msg:
            findings.append(_finding("GL202", f"{msg} inside jitted code", path, node.lineno))
    return findings


def _rule_impure_in_jit(index: _ModuleIndex, path: str) -> list[Finding]:
    """GL204: wall-clock / stdlib-random calls inside jit contexts."""
    findings = []
    for node in ast.walk(index.tree):
        if not (isinstance(node, ast.Call) and index.in_jit_context(node)):
            continue
        canon = index.canonical(node.func)
        if canon is None:
            continue
        hit = None
        if canon in {f"time.{f}" for f in _IMPURE_TIME_FUNCS}:
            hit = f"{canon}() is baked in at trace time"
        elif canon.startswith("random.") or canon.startswith("numpy.random."):
            hit = f"{canon}() draws host randomness once, at trace time"
        if hit:
            findings.append(_finding("GL204", f"{hit} inside jitted code", path, node.lineno))
    return findings


# GL205(a): write-call shapes whose path operand we inspect for live
# checkpoint-directory literals
_WRITE_METHODS = frozenset({"write_text", "write_bytes"})
_WRITE_FUNCS = frozenset({"pickle.dump", "json.dump", "numpy.save", "numpy.savez"})
_ATOMIC_PUBLISH_CALLS = frozenset({
    "os.replace", "os.rename", "shutil.move",
})
_CKPT_PATH_SCOPE = ("resilience", "checkpoint")  # GL205(b) file-path scope


def _string_constants(node) -> Iterable[str]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            yield sub.value


def _statics_of_jit_call(jit_call: ast.Call, fn) -> set:
    """Parameter names of ``fn`` marked static by a ``jax.jit(...)`` call's
    literal ``static_argnums`` / ``static_argnames``."""
    a = fn.args
    positional = [p.arg for p in (*a.posonlyargs, *a.args)]
    names: set = set()
    for kw in jit_call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            idxs = []
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                idxs = [v.value]
            elif isinstance(v, (ast.Tuple, ast.List)):
                idxs = [
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, int)
                ]
            names.update(positional[i] for i in idxs if 0 <= i < len(positional))
        elif kw.arg == "static_argnames":
            v = kw.value
            elts = [v] if isinstance(v, ast.Constant) else (
                v.elts if isinstance(v, (ast.Tuple, ast.List)) else []
            )
            names.update(
                e.value for e in elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            )
    return names


# shape-constructing calls GL305 watches: a traced-shape value flowing into
# one of these re-specializes the program per input shape
_SHAPE_CONSUMER_FUNCS = frozenset({
    "jax.numpy.arange", "jax.numpy.zeros", "jax.numpy.ones", "jax.numpy.full",
    "jax.numpy.broadcast_to", "jax.lax.iota",
})
_SHAPE_CONSUMER_METHODS = frozenset({"reshape", "broadcast_to"})


def _rule_shape_dependent_trace(index: _ModuleIndex, path: str) -> list[Finding]:
    """GL305: ``arg.shape[i]`` of a non-static jit argument flowing directly
    into a shape-constructing call inside jitted code — every distinct input
    shape is a fresh compile (the mid-traffic recompile cause the serving
    bucket ladder exists to remove).  Only the DIRECT flow is flagged: a
    shape read bound to a local first is the documented miss (and routing
    the width through a pinned bucket constant is the fix either way)."""
    # parameter names each function has marked static, from its decorator
    # or any jax.jit(fn_name, static_...) binding in the module
    statics: dict[int, set] = {}
    for fn in index.functions:
        s: set = set()
        for dec in fn.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            if index.canonical(dec.func) in ("jax.jit", "jax.pmap"):
                s |= _statics_of_jit_call(dec, fn)
            elif (index.canonical(dec.func) in ("functools.partial", "partial")
                    and dec.args
                    and index.canonical(dec.args[0]) in ("jax.jit", "jax.pmap")):
                s |= _statics_of_jit_call(dec, fn)
        statics[id(fn)] = s
    by_name: dict[str, list] = {}
    for fn in index.functions:
        by_name.setdefault(fn.name, []).append(fn)
    for node in ast.walk(index.tree):
        if index._is_jit_call(node) and node.args:
            for fn in by_name.get(_dotted(node.args[0]) or "", []):
                statics[id(fn)].update(_statics_of_jit_call(node, fn))

    findings = []
    for node in ast.walk(index.tree):
        if not (isinstance(node, ast.Call) and index.in_jit_context(node)):
            continue
        canon = index.canonical(node.func)
        is_method = (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _SHAPE_CONSUMER_METHODS
        )
        if canon not in _SHAPE_CONSUMER_FUNCS and not is_method:
            continue
        fn = index.enclosing_function(node)
        a = fn.args
        params = {p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)}
        fn_statics = statics.get(id(fn), set())
        flagged = False
        for arg in (*node.args, *[kw.value for kw in node.keywords]):
            if flagged:
                break
            for sub in ast.walk(arg):
                if (
                    isinstance(sub, ast.Subscript)
                    and isinstance(sub.value, ast.Attribute)
                    and sub.value.attr == "shape"
                    and isinstance(sub.value.value, ast.Name)
                    and sub.value.value.id in params
                    and sub.value.value.id not in fn_statics
                ):
                    target = canon if canon in _SHAPE_CONSUMER_FUNCS else (
                        f".{node.func.attr}()"
                    )
                    findings.append(
                        _finding(
                            "GL305",
                            f"`{sub.value.value.id}.shape[...]` flows into "
                            f"{target} inside jitted code and "
                            f"`{sub.value.value.id}` is not static: the "
                            "program re-specializes (recompiles) per input "
                            "shape",
                            path, node.lineno,
                        )
                    )
                    flagged = True
                    break
    return findings


def _walk_same_frame(root):
    """``ast.walk`` that does not descend into nested function/lambda
    bodies: their code runs when the function is CALLED, not where it is
    defined, so a statement inside one is not executed by the enclosing
    loop iteration."""
    frame_nodes = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
    yield root
    stack = [] if isinstance(root, frame_nodes) else [root]
    while stack:
        for child in ast.iter_child_nodes(stack.pop()):
            yield child
            if not isinstance(child, frame_nodes):
                stack.append(child)


def _rule_jit_in_hot_loop(index: _ModuleIndex, path: str) -> list[Finding]:
    """GL306: a ``jax.jit(...)`` call expression constructed inside a
    ``for``/``while`` body — a fresh wrapper (and jit cache) every
    iteration.  Loop ``else`` blocks run once and stay quiet; a ``while``
    test is evaluated per iteration and counts.  A jit inside a function
    merely *defined* in the loop runs at call time, not per iteration, and
    stays quiet."""
    findings = []
    seen: set = set()
    for node in ast.walk(index.tree):
        if not isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            continue
        roots = list(node.body)
        if isinstance(node, ast.While):
            roots.append(node.test)
        for root in roots:
            for sub in _walk_same_frame(root):
                if (
                    isinstance(sub, ast.Call)
                    and index.canonical(sub.func) in ("jax.jit", "jax.pmap")
                    and id(sub) not in seen
                ):
                    seen.add(id(sub))
                    findings.append(
                        _finding(
                            "GL306",
                            f"{index.canonical(sub.func)}(...) constructed "
                            "inside a loop body: a fresh jit wrapper (and "
                            "cache) every iteration — the program recompiles "
                            "per pass",
                            path, sub.lineno,
                        )
                    )
    return findings


def _rule_checkpoint_atomicity(index: _ModuleIndex, path: str) -> list[Finding]:
    """GL205: non-atomic checkpoint writes + swallowed exceptions on the
    save/restore spine.

    (a) A write call — ``open(p, "w"/"wb"/"a"...)``, ``*.write_text``/
    ``write_bytes``, ``pickle.dump``/``json.dump``/``np.save*`` — whose
    *path expression* names a live checkpoint directory (a string literal
    containing ``checkpoint_`` without ``.tmp``, directly or through a
    one-hop local assignment) is flagged unless the enclosing function also
    performs an atomic publish (``os.replace``/``os.rename``/
    ``shutil.move``).  The write-into-tmp-then-replace idiom
    (``checkpointing._finalize_checkpoint``) passes both ways.

    (b) ``except``/``except Exception``/``except BaseException`` whose body
    is exactly ``pass``, in modules whose path mentions resilience or
    checkpoint: on this spine a swallowed failure *is* data loss.
    """
    findings: list[Finding] = []

    # -- (a) non-atomic writes into live checkpoint paths -------------------
    def has_live_ckpt_literal(expr, scope) -> bool:
        def live(s: str) -> bool:
            return "checkpoint_" in s and ".tmp" not in s

        if any(live(s) for s in _string_constants(expr)):
            return True
        # one-hop resolution: `d = f".../checkpoint_{i}"; open(d / "x", "wb")`
        names = {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}
        if not names:
            return False
        for node in ast.walk(scope):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id in names \
                    and any(live(s) for s in _string_constants(node.value)):
                return True
        return False

    def publishes_atomically(scope) -> bool:
        for node in ast.walk(scope):
            if isinstance(node, ast.Call):
                canon = index.canonical(node.func)
                if canon in _ATOMIC_PUBLISH_CALLS:
                    return True
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("replace", "rename")
                    and not isinstance(node.func.value, ast.Constant)
                    and len(node.args) == 1
                    and not node.keywords
                ):
                    # Path.replace(target) / Path.rename(target): exactly one
                    # positional argument — which also keeps the 2-argument
                    # str.replace(old, new) path-mangling idiom from reading
                    # as an atomic publish
                    return True
        return False

    for node in ast.walk(index.tree):
        if not isinstance(node, ast.Call):
            continue
        path_expr = None
        canon = index.canonical(node.func)
        if canon == "open" and node.args:
            mode = ""
            if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant):
                mode = str(node.args[1].value)
            for kw in node.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                    mode = str(kw.value.value)
            if any(m in mode for m in ("w", "a", "x", "+")):
                path_expr = node.args[0]
        elif isinstance(node.func, ast.Attribute) and node.func.attr in _WRITE_METHODS:
            path_expr = node.func.value
        elif canon in _WRITE_FUNCS and len(node.args) >= 2:
            path_expr = node.args[1] if canon in ("pickle.dump", "json.dump") else node.args[0]
        if path_expr is None:
            continue
        scope = index.enclosing_function(node) or index.tree
        if has_live_ckpt_literal(path_expr, scope) and not publishes_atomically(scope):
            findings.append(
                _finding(
                    "GL205",
                    "write into a live `checkpoint_*` path with no atomic "
                    "publish (os.replace) in scope — a crash mid-write "
                    "leaves a directory that looks like a checkpoint",
                    path, node.lineno,
                )
            )

    # -- (b) swallowed exceptions on the resilience/checkpoint spine --------
    posix = path.replace("\\", "/").lower()
    if any(tok in posix for tok in _CKPT_PATH_SCOPE):
        for node in ast.walk(index.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            broad = node.type is None or _dotted(node.type) in (
                "Exception", "BaseException",
            )
            body_is_pass = len(node.body) == 1 and isinstance(node.body[0], ast.Pass)
            if broad and body_is_pass:
                findings.append(
                    _finding(
                        "GL205",
                        "bare `except"
                        + (f" {_dotted(node.type)}" if node.type is not None else "")
                        + ": pass` on the checkpoint/resilience spine — a "
                        "swallowed save/restore failure reads as success",
                        path, node.lineno,
                    )
                )
    return findings


# GL109: host clocks whose deltas bracket async-dispatched work
_TIMING_CLOCKS = frozenset({
    "time.perf_counter", "time.monotonic", "time.time", "time.process_time",
})
# calls that force device execution to complete (or read a concrete value)
_MATERIALIZE_FUNCS = frozenset({
    "jax.block_until_ready", "jax.device_get", "float", "int", "bool",
    "numpy.asarray", "numpy.array", "numpy.testing.assert_allclose",
})
_MATERIALIZE_METHODS = frozenset({"block_until_ready", "item", "tolist"})


def _scope_nodes(scope):
    """Every node in ``scope``'s own frame (module or one function body) —
    nested function/lambda bodies excluded, they run when called."""
    for stmt in scope.body:
        yield from _walk_same_frame(stmt)


def _rule_timing_without_block(index: _ModuleIndex, path: str) -> list[Finding]:
    """GL109 (INFO hint): ``perf_counter()`` deltas bracketing a jitted
    call with no ``block_until_ready()``/materialization in between — jax
    dispatch is async, so the delta measured enqueue time, not compute.

    Shape matched per frame: ``t0 = time.perf_counter()`` ... a call of a
    ``jax.jit``-bound name (or a jit-decorated function, or a direct
    ``jax.jit(f)(x)``) ... ``<expr> - t0`` with no materializing call
    (``jax.block_until_ready``/``float``/``np.asarray``/``.item()``/...)
    between the LAST jitted call and the delta.  The timed-loop
    idiom (jitted steps, then ``float(loss)`` + ``block_until_ready``,
    then the closing clock read) passes clean.  Known miss: timing through
    a method call (``engine.run(...)``) or a helper bound outside the
    module — only bare names the module itself jit-binds are tracked."""
    findings: list[Finding] = []
    jit_fn_names = {
        fn.name for fn in index.functions if id(fn) in index.jit_contexts
    }
    scopes: list = [index.tree] + list(index.functions)
    for scope in scopes:
        clock_assigns: dict[str, list[int]] = {}
        deltas: list[tuple[int, str]] = []
        jit_lines: list[int] = []
        mat_lines: list[int] = []
        for node in _scope_nodes(scope):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and index.canonical(node.value.func) in _TIMING_CLOCKS
            ):
                clock_assigns.setdefault(node.targets[0].id, []).append(node.lineno)
            if (
                isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)
                and isinstance(node.right, ast.Name)
            ):
                deltas.append((node.lineno, node.right.id))
            if isinstance(node, ast.Call):
                canon = index.canonical(node.func)
                if canon in _MATERIALIZE_FUNCS or (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _MATERIALIZE_METHODS
                ):
                    mat_lines.append(node.lineno)
                func = node.func
                if (
                    isinstance(func, ast.Name)
                    and (func.id in index.jit_bound_names or func.id in jit_fn_names)
                ) or (isinstance(func, ast.Call) and index._is_jit_call(func)):
                    jit_lines.append(node.lineno)
        for lineno, name in deltas:
            starts = [l for l in clock_assigns.get(name, []) if l < lineno]
            if not starts:
                continue
            t0_line = max(starts)
            bracketed = [l for l in jit_lines if t0_line < l < lineno]
            if not bracketed:
                continue
            last_jit = max(bracketed)
            if any(last_jit <= l <= lineno for l in mat_lines):
                continue
            findings.append(
                _finding(
                    "GL109",
                    f"clock delta over `{name}` brackets the jitted call at "
                    f"line {last_jit} with no block_until_ready()/"
                    "materialization in between: jax dispatch is async, so "
                    "this measures host enqueue time, not device compute",
                    path, lineno,
                )
            )
    return findings


# GL206: calls that DRAIN a pending async snapshot (or otherwise fence the
# background read) — any of these between the initiator and the donating
# call closes the aliasing window
_SNAPSHOT_DRAIN_NAMES = frozenset({
    "wait_for_checkpoint",
    "wait_for_pending_checkpoint",
    "wait_until_finished",
    "block_until_ready",
    "join",
    "end_training",
})


def _rule_snapshot_donation_race(index: _ModuleIndex, path: str) -> list[Finding]:
    """GL206: a TrainState name handed to an async checkpoint initiator
    (``async_save=True``) is later passed in a DONATED position with no
    rebind or drain in between.

    The background write may still be reading the very buffers the compiled
    program then overwrites in place — the snapshot-aliasing race the
    sharding-preserving copy in ``save_accelerator_state`` (and the
    ``np.array(copy=True)`` in ``peer_ckpt._host_view``) exists to close.
    User code that starts its OWN async write and then donates the same
    state re-opens it.  Rebinding the name (``state, m = step(state, b)``
    consumed by a later save) or any drain call
    (:data:`_SNAPSHOT_DRAIN_NAMES`) between the two closes the window."""
    findings: list[Finding] = []
    scopes: list = [index.tree] + list(index.functions)
    for scope in scopes:
        own = (
            lambda n: index.enclosing_function(n) is scope
            if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef))
            else index.enclosing_function(n) is None
        )
        initiators: list[tuple[ast.Call, set]] = []  # (call, snapshotted names)
        donators: list[tuple[ast.Call, list]] = []   # (call, donated names)
        drains: list[int] = []
        rebinds: dict[str, list[int]] = {}
        for node in ast.walk(scope):
            if isinstance(node, ast.Name) and own(node):
                aug = isinstance(index._parent.get(id(node)), ast.AugAssign)
                if isinstance(node.ctx, (ast.Store, ast.Del)) and not aug:
                    rebinds.setdefault(node.id, []).append(node.lineno)
            if not (isinstance(node, ast.Call) and own(node)):
                continue
            fname = (node.func.attr if isinstance(node.func, ast.Attribute)
                     else node.func.id if isinstance(node.func, ast.Name)
                     else None)
            if fname in _SNAPSHOT_DRAIN_NAMES:
                drains.append(node.lineno)
                continue
            if any(kw.arg == "async_save"
                   and isinstance(kw.value, ast.Constant)
                   and kw.value.value is True
                   for kw in node.keywords):
                names = {a.id for a in node.args if isinstance(a, ast.Name)}
                names |= {kw.value.id for kw in node.keywords
                          if kw.arg != "async_save"
                          and isinstance(kw.value, ast.Name)}
                if names:
                    initiators.append((node, names))
                continue
            donated: tuple[int, ...] = ()
            if isinstance(node.func, ast.Name) and node.func.id in index.donated_callables:
                donated = index.donated_callables[node.func.id]
            elif isinstance(node.func, ast.Call) and index._is_jit_call(node.func):
                donated = _donate_positions(node.func)
            dnames = [
                node.args[i].id
                for i in donated
                if i < len(node.args) and isinstance(node.args[i], ast.Name)
            ]
            if dnames:
                donators.append((node, dnames))
        for init, snap_names in initiators:
            init_end = getattr(init, "end_lineno", init.lineno) or init.lineno
            for call, dnames in sorted(donators, key=lambda c: c[0].lineno):
                if call.lineno <= init_end:
                    continue
                hot = [n for n in dnames if n in snap_names]
                if not hot:
                    continue
                if any(init_end < l <= call.lineno for l in drains):
                    break  # drained: this and every later donation is safe
                name = hot[0]
                if any(init_end < l < call.lineno
                       for l in rebinds.get(name, [])):
                    continue  # rebound: the snapshotted buffer is detached
                findings.append(
                    _finding(
                        "GL206",
                        f"`{name}` was handed to an async checkpoint at line "
                        f"{init.lineno} (async_save=True) and is donated here "
                        "with no drain or rebind in between: the background "
                        "write may still be reading the buffers the compiled "
                        "program overwrites in place — drain "
                        "(wait_for_checkpoint) or snapshot-copy first",
                        path, call.lineno,
                    )
                )
                break  # one finding per initiator keeps the report readable
    return findings


_ALL_RULES = (
    _rule_donated_reuse,
    _rule_host_sync,
    _rule_impure_in_jit,
    _rule_checkpoint_atomicity,
    _rule_shape_dependent_trace,
    _rule_jit_in_hot_loop,
    _rule_timing_without_block,
    _rule_snapshot_donation_race,
)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def lint_source(source: str, path: str = "<string>") -> list[Finding]:
    """All AST findings for one module's source (suppressions not yet
    applied — :func:`lint_paths` resolves them against the real file)."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [
            _finding("GL002", f"unparseable module: {e.msg}", path, e.lineno or 1)
        ]
    index = _ModuleIndex(tree)
    findings = []
    for rule_fn in _ALL_RULES:
        findings.extend(rule_fn(index, path))
    # GL001 contract: EVERY rationale-less marker is reported, including
    # stale ones that no longer match any finding (apply_suppressions
    # dedupes against these when a bare marker does suppress something)
    for lineno, text in enumerate(source.splitlines(), start=1):
        parsed = parse_marker(text)
        if parsed is not None and parsed[1] is None:
            findings.append(
                _finding(
                    "GL001",
                    "suppression marker without a rationale "
                    "(add `-- <why this hazard is intentional>`)",
                    path, lineno,
                )
            )
    return findings


def iter_python_files(paths: Sequence, excludes: Sequence[str] = DEFAULT_EXCLUDES):
    """``*.py`` files under ``paths``.  ``excludes`` (path substrings) and
    :data:`DEFAULT_EXCLUDE_DIRS` (vendored/generated directory names) apply
    only to directory sweeps — a file named explicitly is always yielded,
    even if missing (so :func:`lint_paths` can report the bad target loudly
    instead of letting a typo'd CI path pass as a clean run)."""
    for p in paths:
        p = Path(p)
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if DEFAULT_EXCLUDE_DIRS.intersection(f.parts):
                    continue
                if any(ex in f.as_posix() for ex in excludes):
                    continue
                yield f
        else:
            yield p


def resolve_targets(
    paths: Sequence,
    excludes: Sequence[str] = DEFAULT_EXCLUDES,
) -> tuple[list, list[Finding]]:
    """The ONE target resolver every CLI surface shares (``lint`` and
    ``preflight``): expand ``paths`` to ``(readable sources, GL002 findings
    for every explicitly named target that does not exist or cannot be
    read)``.  Factored so a typo'd CI path fails loudly in every command
    that takes paths — never a silently skipped target passing as clean.

    Returns ``[(Path, source_text), ...]`` plus the error findings.
    """
    sources: list = []
    findings: list[Finding] = []
    for f in iter_python_files(paths, excludes):
        try:
            sources.append((f, f.read_text()))
        except (OSError, UnicodeDecodeError) as e:
            findings.append(_finding("GL002", f"unreadable target: {e}", str(f), 1))
    return sources, findings


def lint_paths(
    paths: Sequence,
    excludes: Sequence[str] = DEFAULT_EXCLUDES,
) -> Report:
    """Lint every ``*.py`` under ``paths`` (files or directories), resolve
    inline suppressions, and return the combined :class:`Report`."""
    sources, findings = resolve_targets(paths, excludes)
    for f, source in sources:
        findings.extend(lint_source(source, str(f)))
    return Report(apply_suppressions(findings))
