"""JAX purity lint — side effects inside jit/pmap/shard_map-traced code.

A traced function runs ONCE per compilation, not once per step: a
``print`` silently stops printing, ``time.time()`` freezes at trace
time, host RNG becomes a compile-time constant, ``.item()``/
``np.asarray`` force a device→host sync per call (or leak a tracer),
and mutating captured Python state from inside the trace is a
correctness bug that only shows up after a cache hit. This pass finds
jit roots and walks their call graphs statically:

- Roots: arguments of ``jit``/``pmap``/``shard_map``/``pallas_call``
  calls (by name, lambda, or ``functools.partial(f, ...)`` — including
  a local alias ``k = partial(f, ...); pallas_call(k, ...)`` and a
  static gate ``train_fn = plane_fn if use_plane else tree_fn``, whose
  BOTH branches are roots — the stacked/donated step builders pick
  their traced body this way) and functions decorated with them.
- Expansion: callees by bare name or ``self.<name>`` resolve within the
  same module; bare names also resolve to uniquely-named top-level
  functions elsewhere in the scanned set (the ``ops.losses`` functions
  called from jitted learner bodies). ``custom_vjp``/``defvjp`` are NOT
  wrappers (vjp rules legitimately build ``float0`` zeros with numpy),
  and flax ``nn.Module.__call__`` is not treated as a root.
- Rules inside traced scope: ``purity.print``, ``purity.logging``,
  ``purity.time``, ``purity.host-rng`` (``random``/``np.random``),
  ``purity.host-sync`` (``.item()``, ``np.asarray``/``np.array``), and
  ``purity.captured-write`` (assignment through an attribute/subscript
  whose base is not a local of the function or of a traced function it
  is nested in, ``global``/``nonlocal``).

Scope: ``parallel/``, ``ops/``, ``models/``.
"""

from __future__ import annotations

import ast
import os

from distributed_deep_q_tpu.analysis.core import (
    Finding, Source, call_name, dotted, load_sources)

SCAN_DIRS = ("distributed_deep_q_tpu/parallel",
             "distributed_deep_q_tpu/ops",
             "distributed_deep_q_tpu/models")

JIT_WRAPPERS = {"jit", "pmap", "shard_map", "pallas_call"}

_TIME_PREFIXES = ("time.", "datetime.")
_RNG_PREFIXES = ("random.", "np.random.", "numpy.random.")
_HOST_SYNC = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                "critical"}

_FuncNode = ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda


def _last(name: str | None) -> str | None:
    return name.rsplit(".", 1)[-1] if name else None


def _unwrap_partial(node: ast.AST) -> ast.AST:
    """``functools.partial(f, ...)`` / ``partial(f, ...)`` → ``f``."""
    if isinstance(node, ast.Call) and _last(call_name(node)) == "partial" \
            and node.args:
        return node.args[0]
    return node


class _ModuleIndex:
    """Function defs of one module, by bare name (a reused name — two
    nested ``step_fn`` builders — maps to ALL its defs; linting an extra
    candidate is over-strict, never unsound), plus which names are
    top-level (eligible for cross-module calls)."""

    def __init__(self, src: Source):
        self.src = src
        self.by_name: dict[str, list[_FuncNode]] = {}
        self.top_level: set[str] = set()
        for node in src.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
            self.by_name.setdefault(node.name, []).append(node)
        for node in src.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.top_level.add(node.name)


def _alias_targets(value: ast.AST) -> list[str]:
    """Function names an assignment RHS can resolve to: a bare ``f``, a
    ``partial(f, ...)``, or a static gate picking between builders —
    ``train_fn = plane_train_fn if use_plane else tree_train_fn`` (the
    stacked/donated step builders select their traced body this way);
    both branches are roots."""
    value = _unwrap_partial(value)
    if isinstance(value, ast.IfExp):
        return _alias_targets(value.body) + _alias_targets(value.orelse)
    if isinstance(value, ast.Name):
        return [value.id]
    return []


def _local_aliases(src: Source) -> dict[str, list[str]]:
    """``x = f`` / ``x = partial(f, ...)`` / ``x = f if gate else g``
    anywhere in the module → {x: [f, ...]} for resolving wrapper
    arguments passed by name. The same alias name in different scopes
    (``kernel = partial(...)`` in two builders) keeps every target."""
    out: dict[str, list[str]] = {}
    for node in src.nodes(ast.Assign):
        if len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            for name in _alias_targets(node.value):
                out.setdefault(node.targets[0].id, []).append(name)
    return out


def _collect_roots(idx: _ModuleIndex) -> list[_FuncNode]:
    roots: list[_FuncNode] = []
    seen: set[int] = set()

    def add(node: ast.AST | None) -> None:
        if isinstance(node, _FuncNode) and id(node) not in seen:
            seen.add(id(node))
            roots.append(node)

    aliases = _local_aliases(idx.src)

    def resolve(arg: ast.AST) -> None:
        arg = _unwrap_partial(arg)
        if isinstance(arg, ast.Lambda):
            add(arg)
        elif isinstance(arg, ast.IfExp):
            resolve(arg.body)
            resolve(arg.orelse)
        elif isinstance(arg, ast.Name):
            for name in aliases.get(arg.id, [arg.id]):
                for fn in idx.by_name.get(name, []):
                    add(fn)

    for node in idx.src.nodes(ast.Call):
        if _last(call_name(node)) in JIT_WRAPPERS and node.args:
            resolve(node.args[0])
    for node in idx.src.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = _last(dotted(target))
            if name in JIT_WRAPPERS:
                add(node)
            elif name == "partial" and isinstance(dec, ast.Call) \
                    and dec.args \
                    and _last(dotted(dec.args[0])) in JIT_WRAPPERS:
                add(node)
    return roots


def _expand(roots: list[_FuncNode], idx: _ModuleIndex,
            global_index: dict[str, tuple[_ModuleIndex, _FuncNode]],
            ) -> list[tuple[_ModuleIndex, _FuncNode]]:
    """Transitive closure of statically-resolvable callees."""
    work = [(idx, r) for r in roots]
    seen = {id(r) for r in roots}
    out: list[tuple[_ModuleIndex, _FuncNode]] = []
    while work:
        mod, fn = work.pop()
        out.append((mod, fn))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name is None:
                continue
            targets: list[tuple[_ModuleIndex, _FuncNode]] = []
            parts = name.split(".")
            if len(parts) == 1:
                local = mod.by_name.get(parts[0], [])
                if local:
                    targets = [(mod, f) for f in local]
                elif parts[0] in global_index:
                    targets = [global_index[parts[0]]]
            elif len(parts) == 2 and parts[0] in ("self", "cls"):
                targets = [(mod, f)
                           for f in mod.by_name.get(parts[1], [])]
            for target in targets:
                if id(target[1]) not in seen:
                    seen.add(id(target[1]))
                    work.append(target)
    return out


def _scope_locals(fn: _FuncNode) -> set[str]:
    """Names bound inside this scope (args + assignments), not
    descending into nested function scopes."""
    names: set[str] = set()
    a = fn.args
    for arg in [*a.posonlyargs, *a.args, *a.kwonlyargs]:
        names.add(arg.arg)
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)

    def collect_target(t: ast.AST) -> None:
        # only Store-context names BIND: in ``stats["k"] = v`` the base
        # ``stats`` is a Load and stays captured, not local
        for n in ast.walk(t):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                names.add(n.id)

    def handle(node: ast.AST) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            names.add(node.name)
            return  # nested scope: only its name binds here
        if isinstance(node, ast.Lambda):
            return
        if isinstance(node, ast.Assign):
            for t in node.targets:
                collect_target(t)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign,
                               ast.For, ast.AsyncFor)):
            collect_target(node.target)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if item.optional_vars is not None:
                    collect_target(item.optional_vars)
        elif isinstance(node, ast.NamedExpr):
            collect_target(node.target)
        elif isinstance(node, ast.comprehension):
            collect_target(node.target)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            names.add(node.name)
        for child in ast.iter_child_nodes(node):
            handle(child)

    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        handle(stmt)
    return names


def _base_name(node: ast.AST) -> str | None:
    """Leftmost Name of an Attribute/Subscript chain."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _lint_calls(fn: _FuncNode, src: Source, out: list[Finding]) -> None:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name is None:
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                src.finding("purity.host-sync", node,
                            ".item() forces a device->host sync inside a "
                            "traced function", out)
            continue
        last = _last(name)
        if name == "print":
            src.finding("purity.print", node,
                        "print() inside a traced function runs only at "
                        "trace time", out)
        elif name.startswith("logging.") or (
                "." in name and name.split(".", 1)[0] in ("log", "logger")
                and last in _LOG_METHODS):
            src.finding("purity.logging", node,
                        f"{name}() inside a traced function runs only at "
                        "trace time", out)
        elif name.startswith(_TIME_PREFIXES):
            src.finding("purity.time", node,
                        f"{name}() is a trace-time constant inside jit", out)
        elif name.startswith(_RNG_PREFIXES):
            src.finding("purity.host-rng", node,
                        f"{name}() is host RNG — a trace-time constant "
                        "inside jit (use jax.random)", out)
        elif name in _HOST_SYNC or last == "item":
            src.finding("purity.host-sync", node,
                        f"{name}() forces a device->host sync / tracer "
                        "leak inside a traced function", out)


def _lint_writes(fn: _FuncNode, src: Source, out: list[Finding],
                 enclosing: frozenset[str] = frozenset()) -> None:
    # a nested function sees its enclosing traced function's names: a
    # write through one of THOSE (a Pallas kernel's ``@pl.when`` body
    # storing to the kernel's own ref) is no more captured state than the
    # same write one level up
    locals_ = _scope_locals(fn) | enclosing
    body = fn.body if isinstance(fn.body, list) else [fn.body]

    def check_target(t: ast.AST, node: ast.AST) -> None:
        if isinstance(t, (ast.Attribute, ast.Subscript)):
            base = _base_name(t)
            if base is not None and (base in ("self", "cls")
                                     or base not in locals_):
                src.finding("purity.captured-write", node,
                            f"mutation of captured state {base!r} inside a "
                            "traced function (effect happens once, at "
                            "trace time)", out)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for el in t.elts:
                check_target(el, node)

    def handle(node: ast.AST) -> None:
        if isinstance(node, _FuncNode):
            _lint_writes(node, src, out, frozenset(locals_))
            return
        if isinstance(node, ast.Assign):
            for t in node.targets:
                check_target(t, node)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            check_target(node.target, node)
        elif isinstance(node, (ast.Global, ast.Nonlocal)):
            kw = "global" if isinstance(node, ast.Global) else "nonlocal"
            src.finding("purity.captured-write", node,
                        f"{kw} statement inside a traced function", out)
        for child in ast.iter_child_nodes(node):
            handle(child)

    for stmt in body:
        handle(stmt)


def check_sources(sources: list[Source]) -> list[Finding]:
    out: list[Finding] = []
    indexes = [_ModuleIndex(s) for s in sources]
    global_index: dict[str, tuple[_ModuleIndex, _FuncNode]] = {}
    ambiguous: set[str] = set()
    for idx in indexes:
        for name in idx.top_level:
            fns = idx.by_name.get(name, [])
            if len(fns) != 1:
                continue  # reused within its own module: not a unique target
            if name in global_index:
                ambiguous.add(name)
            global_index[name] = (idx, fns[0])
    for name in ambiguous:
        global_index.pop(name, None)

    linted: set[int] = set()
    for idx in indexes:
        roots = _collect_roots(idx)
        for mod, fn in _expand(roots, idx, global_index):
            if id(fn) in linted:
                continue
            linted.add(id(fn))
            _lint_calls(fn, mod.src, out)
            _lint_writes(fn, mod.src, out)
    # a nested def can be linted via its parent's subtree AND via call
    # expansion — keep one copy of each finding
    uniq: dict[tuple, Finding] = {}
    for f in out:
        uniq.setdefault((f.rule, f.path, f.line, f.message), f)
    return list(uniq.values())


def check(repo_root: str) -> list[Finding]:
    from distributed_deep_q_tpu.analysis.core import iter_py_files
    paths: list[str] = []
    for d in SCAN_DIRS:
        full = os.path.join(repo_root, d)
        if os.path.isdir(full):
            paths.extend(iter_py_files(full))
    return check_sources(load_sources(repo_root, sorted(set(paths))))
